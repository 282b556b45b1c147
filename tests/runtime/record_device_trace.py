"""Record the small device trace that ``tests/runtime/test_device_time.py``
and ``chipbench/tests/test_device_parts.py`` read.  Run once on the chip
(``python3 tests/runtime/record_device_trace.py <out dir>``); the result is
committed as ``tests/runtime/data/toy_decode.xplane.pb`` (stripped to the
lines ``XLA Ops`` and ``XLA Modules`` and the capture's marker: 88 KB of
853) and ``toy_decode.hlo.json.gz`` (the optimised HLO texts of the
programs that registered themselves, by the name the profiler gives their
runs).

A two-layer ``GPTModel`` of OPT-1.3B's widths behind a ``Generator``: inside one capture
(``telemetry.trace.start_capture`` / ``stop_capture``) one dense prefill of
two rows and six decode ticks, each waited for."""
import dataclasses
import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def strip(src: str, dst: str):
    """Copy a ``.xplane.pb`` with only what ``device_time.read_profile``
    reads: each TPU plane's ``XLA Ops`` and ``XLA Modules`` (names and
    times, no stats) and the capture's marker on the host plane.  Needs
    tensorflow's copy of the trace's schema; without it the file is copied
    whole."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        shutil.copy(src, dst)
        return
    from alpa_tpu.telemetry.trace import CAPTURE_MARKER
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    kept = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [line for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")]
            wanted = {e.metadata_id for line in lines for e in line.events}
        elif plane.name == "/host:CPU":
            wanted = {k for k, m in plane.event_metadata.items()
                      if m.name == CAPTURE_MARKER}
            lines = []
            for line in plane.lines:
                events = [e for e in line.events if e.metadata_id in wanted]
                if events:
                    del line.events[:]
                    line.events.extend(events)
                    lines.append(line)
        else:
            continue
        for line in lines:
            for event in line.events:
                del event.stats[:]
        del plane.lines[:]
        plane.lines.extend(lines)
        for key in list(plane.event_metadata):
            if key not in wanted:
                del plane.event_metadata[key]
            else:
                meta = plane.event_metadata[key]
                del meta.stats[:]
                meta.ClearField("display_name")
                meta.ClearField("metadata")
        plane.stat_metadata.clear()
        del plane.stats[:]
        kept.append(plane)
    del space.planes[:]
    space.planes.extend(kept)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def main(out_dir: str):
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import config_from_opt_spec, init_gpt_real
    from alpa_tpu.serve.generation import Generator
    from alpa_tpu.telemetry import device_time
    from alpa_tpu.telemetry import trace as ttrace

    # OPT-1.3B's widths, two layers (at toy widths the compiler prefetches
    # whole weights with copies of its own, and most of a decode is those)
    cfg = dataclasses.replace(
        config_from_opt_spec("opt-1.3b", dtype=jnp.bfloat16), num_layers=2)
    model, params = init_gpt_real(cfg, 1)
    gen = Generator(model, params, cfg, batch_size=2, prompt_buckets=[32])
    rows = 2
    ids = jnp.ones((rows, 32), jnp.int32)
    lengths = jnp.asarray([20, 31], jnp.int32)

    def run(ticks):
        logits, caches = gen._prefill(gen.params, ids, None, lengths)
        index = lengths
        for _ in range(ticks):
            token = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            logits, caches, _ = gen._decode(gen.params, token, index, caches)
            index = index + 1
            jax.block_until_ready(logits)

    run(2)          # compile, and start the profiler once for nothing
    trace_dir = os.path.join(out_dir, "capture")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ttrace.start_capture(trace_dir)
    ttrace.stop_capture()
    shutil.rmtree(trace_dir, ignore_errors=True)
    ttrace.start_capture(trace_dir)
    run(6)
    capture = ttrace.stop_capture()
    os.makedirs(out_dir, exist_ok=True)
    strip(capture.xplane_path(),
          os.path.join(out_dir, "toy_decode.xplane.pb"))
    texts = {name: [entry[1](entry[0]()) for entry in entries.values()
                    if entry[0]() is not None]
             for name, entries in device_time._PROGRAMS.items()}
    with gzip.open(os.path.join(out_dir, "toy_decode.hlo.json.gz"),
                   "wt") as f:
        json.dump(texts, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"offset_us": capture.offset_us(),
                      "device_time": capture.device_time()},
                     default=str, indent=1))
    for name in ("toy_decode.xplane.pb", "toy_decode.hlo.json.gz"):
        print(name, os.path.getsize(os.path.join(out_dir, name)), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
