"""Record the small device trace the tests of ``chipbench.xplane`` read.
Run once on the chip (``python3 chipbench/tests/record_trace.py <out.pb>``);
the result is committed as ``chipbench/tests/data/small_trace.xplane.pb``.

Three "steps" of one small jitted matmul chain, each called under the span
``step_call`` and waited for under ``step_wait``, with a 20 ms sleep under
``pause`` between steps: the device is busy in each step and idle in each
pause, and the pauses are the longest gaps."""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_path: str):
    import jax
    import jax.numpy as jnp
    from chipbench import observe, xplane

    @jax.jit
    def work(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.full((2048, 2048), 0.001, jnp.bfloat16)
    work(x).block_until_ready()
    spans = observe.Spans()
    trace_dir = out_path + ".dir"
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    spans.annotate = True
    with spans.span("traced_window"):
        for _ in range(3):
            with spans.span("step_call"):
                y = work(x)
            with spans.span("step_wait"):
                y.block_until_ready()
            with spans.span("pause"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(xplane.find_xplane(trace_dir), out_path)
    shutil.rmtree(trace_dir)
    print(out_path, os.path.getsize(out_path), "bytes")
    try:
        print(xplane.reduce_events(*xplane.read_trace(out_path)))
    except ValueError as e:     # a CPU backend has no TPU plane
        print("no summary:", e)
    data = jax.profiler.ProfileData.from_file(out_path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("   LINE", line.name, len(events),
                  [e.name for e in events[:4]])


if __name__ == "__main__":
    main(sys.argv[1])
