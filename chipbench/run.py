"""Run one cell of the benchmark once and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` (or, for the CPU
rehearsal, of ``chipbench/rehearsal.json``).  Everything that belongs to one
configuration, one traffic mix or one metric is a file found by its name:
``configs/<configuration>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py``, ``references/<reference>.py``,
``drivers/<driver>.py``.  See ``chipbench/README.md``.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, and ``device`` then also holds
``busy_s`` and ``window_s`` of the traced seconds or steps.  Earlier lines
(``{"info": ...}``) are for the record.  Without a TPU, or with another
number of chips than the cell asks for, the run fails and prints no result.

``--trace 2`` is a ``--trace 0`` run followed by a short traced part in the
same process.  Up to the end of the measured window it does what
``--trace 0`` does, and ``correct``, ``attempted``, ``failed`` and the
end-to-end metrics are the window's.  Then it starts and stops the
program's capture once (thrown away), captures ``trace_steps`` more steps
or ``trace_seconds`` more seconds of the same traffic, and prints both
kinds of metric side by side in ``metrics``; ``device`` holds ``busy_s``
and ``window_s`` of the traced part and the memory peak of the whole run.

What a reader is given (``obs``): in every mode ``obs["counters"]`` is the
program's metrics registry at the window's start and end (two
``snapshot()`` dicts).  In ``--trace 2`` the program's spans exist only in
the traced part, so a per-layer metric whose ``source`` is ``program_span``
or ``device_trace`` is handed the traced interval as its ``window`` and
``program_window_us`` (and, serving, every request that streamed in it as
``requests``); every other reader sees the measured window.
"""
import argparse
import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:    # `python3 chipbench/run.py` as well as `-m`
    sys.path.insert(0, ROOT)


def load_module(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``, found by file name: a
    name may hold dots and dashes, which an import statement cannot."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"chipbench has no {kind[:-1]} {name!r}: "
                                f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The reader of a metric: ``metrics/<name>.py``, or, for a quantity
    split by what it moves (``hbm_peak_gb.train``, ``hbm_peak_gb.serve``),
    the one reader of its stem, ``metrics/hbm_peak_gb.py``."""
    stem = name.split(".")[0]
    if stem != name and not os.path.exists(
            os.path.join(HERE, "metrics", name + ".py")):
        name = stem
    return load_module("metrics", name).read


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str):
    """(benchmark, cell, rehearsal?)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return bench, cell, False
    for cell in load_json(HERE, "rehearsal.json")["workloads"]:
        if cell["name"] == name:
            return bench, cell, True
    raise SystemExit(f"chipbench: no cell named {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: int      # 0, 1 (a traced run of its own) or 2 (traced after)
    rehearsal: bool
    spans: Any
    compile_events: Any
    trace_dir: str

    @staticmethod
    def load(kind: str, name: str):
        return load_module(kind, name)

    @staticmethod
    def info(obj: dict):
        print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1, 2),
                        required=True)
    args = parser.parse_args(argv)

    bench, cell, rehearsal = find_cell(args.workload)
    # a rehearsal cell reports the metrics of the cell it stands in for
    reports_as = cell.get("as", cell["name"])
    config = load_json(HERE, "configs", cell["config"] + ".json")

    from chipbench import counters, observe, peaks, stats, traffic
    mix = traffic.load_mix(cell["traffic"])

    import jax
    from alpa_tpu.platform import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    # every program goes to the persistent cache, the many small ones of
    # the serving path too: set-up is paid by every run of every check
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not config.get("compile_cache", True):
        # a configuration whose programs do not survive the cache (the
        # file says why) compiles in every run
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    compile_events = observe.CompileEvents()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not rehearsal:
        if device["platform"] != "tpu":
            raise SystemExit(f"chipbench: cell {cell['name']} needs a TPU; "
                             f"jax found {device}")
        if device["count"] != cell["chips"]:
            raise SystemExit(f"chipbench: cell {cell['name']} needs "
                             f"{cell['chips']} chip(s); jax found {device}")
    chip_peaks = None if rehearsal else peaks.peaks_for(device["kind"])

    ctx = Context(cell=cell, config=config, mix=mix, seed=args.seed,
                  seconds=args.seconds, trace=args.trace,
                  rehearsal=rehearsal, spans=observe.Spans(),
                  compile_events=compile_events,
                  trace_dir=os.path.join(ROOT, ".chipbench_trace",
                                         cell["name"]))
    ctx.info({"info": "start", "cell": cell["name"], "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "device": device, "compile_cache_dir": cache_dir})
    obs = load_module("drivers", config["driver"]).run(ctx)
    obs.update(spans=ctx.spans, compile=compile_events.snapshot(),
               peaks=chip_peaks, chips=len(devices), seconds=args.seconds,
               config=config, mix=mix)

    # the readers of spans and of the device trace see the traced part
    traced_obs = {**obs, **obs["traced"]}
    groups = {0: ["end_to_end"], 1: ["per_layer"],
              2: ["end_to_end", "per_layer"]}[args.trace]
    metrics = {}
    for group in groups:
        for entry in metrics_of(bench, group, reports_as):
            view = traced_obs if group == "per_layer" and entry["source"] \
                in ("program_span", "device_trace") else obs
            value = metric_reader(entry["name"])(view)
            # a reader that finds nothing reports nothing
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}

    # the peak of the whole run: read again after the traced part
    peak = max((m["peak_bytes_in_use"] or 0) for m in obs["memory_run"])
    device["memory_peak_bytes"] = peak
    line = {"correct": obs["correct"], "attempted": obs["attempted"],
            "failed": obs["failed"], "metrics": metrics, "device": device}
    summary = obs.get("device_trace")
    if args.trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        device["busy_s_by_chip"] = summary["busy_s_by_chip"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
        ctx.info({"info": "uncovered", "s_at_s": summary["uncovered"]})
        # each program's runs on the device: [how many, median s, total s]
        ctx.info({"info": "program_runs", **{
            name: [len(secs), secs[len(secs) // 2], sum(secs)]
            for name, secs in sorted(summary["program_runs"].items(),
                                     key=lambda kv: -sum(kv[1]))[:10]}})
    if args.trace:
        # the program's spans of the traced part, by name:
        # [how many, total ms]
        table = {}
        for span in stats.program_spans(traced_obs, ""):
            row = table.setdefault(span["name"], [0, 0.0])
            row[0] += 1
            row[1] += span["dur_us"] / 1e3
        ctx.info({"info": "program_spans", **dict(sorted(
            table.items(), key=lambda kv: -kv[1][1])[:16])})
    # what the program's registry counted over the window
    ctx.info({"info": "counters", **counters.moved(obs)})
    ctx.info({"info": "checks", **obs["checks"]})
    ctx.info({"info": "timers", **obs["timers"],
              "compile": compile_events.snapshot()})
    ctx.info({"info": "memory", "devices": obs["memory"]})
    line.update(cell=cell["name"], seed=args.seed, rehearsal=rehearsal)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
