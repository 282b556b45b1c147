"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors SURVEY.md §4's implication (d)/(e): single-host multi-chip tests
stand in for a pod; compile-only tests need no TPU at all.
"""
import os

# Must run before the first backend use: force an 8-device virtual CPU
# mesh.  Set ALPA_TPU_TEST_ON_TPU=1 to keep the real backend (tests/tpu/).
_on_tpu = os.environ.get("ALPA_TPU_TEST_ON_TPU") == "1"
if not _on_tpu:
    from alpa_tpu.platform import pin_cpu_platform
    pin_cpu_platform(8)
os.environ["ALPA_TPU_TESTING"] = "1"

import pytest  # noqa: E402

import alpa_tpu  # noqa: E402


@pytest.fixture(autouse=True)
def reset_cluster_state():
    yield
    alpa_tpu.shutdown()


@pytest.fixture(autouse=True)
def isolated_compile_cache():
    """Each test gets a fresh, memory-only compile cache: no cross-test
    hit/miss bleed, and a developer's ALPA_TPU_CACHE_DIR never leaks
    persisted solver decisions into (or out of) the test run.  Tests that
    want a disk tier point ``global_config.compile_cache_dir`` at a
    tmp_path and call ``reset_compile_cache()`` themselves."""
    from alpa_tpu.compile_cache import reset_compile_cache
    from alpa_tpu.global_env import global_config
    prev_dir = global_config.compile_cache_dir
    global_config.compile_cache_dir = None
    reset_compile_cache()
    yield
    global_config.compile_cache_dir = prev_dir
    reset_compile_cache()


@pytest.fixture
def checks_the_same_requests():
    """What makes a benchmark driver's toy cell check the same requests
    whatever the machine's load: ``steady(ctx)`` returns ``ctx`` with a
    ``load`` under which ``drivers/serve_lm.py``'s ``_pick`` (every chunked
    serving driver's) is handed, of the requests the window completed,
    the first ``pool_size`` of the closed loop's stream (one walk of the
    mix's pool of sizes; the stream is the seed's, so they are the same
    requests in every run), in the stream's order.  As the drivers call it,
    it draws its picks from a permutation of however many requests the
    window completed, which a loaded machine changes: under the driver's
    six workers the LongCat toy cell so came to check requests whose
    readings its limits do not cover (of 60 checked requests 3 positions
    read over them), and failed one run in three.  The window must
    complete those first requests (three seconds complete some 190
    requests under six workers, where the pool is 16)."""
    def steady(ctx):
        from chipbench import traffic
        load = ctx.load
        stream = traffic.closed_loop(ctx.mix, ctx.seed,
                                     ctx.config["vocab_size"])
        first = [tuple(next(stream)["prompt_ids"])
                 for _ in range(ctx.mix["pool_size"])]

        def of_the_first(pick):
            def picking(done, *args):
                by_prompt = {tuple(rec["prompt_ids"]): rec for rec in done}
                missing = [ids for ids in first if ids not in by_prompt]
                assert not missing, \
                    f"the window did not complete {len(missing)} of the " \
                    f"stream's first {len(first)} requests"
                return pick([by_prompt[ids] for ids in first], *args)
            return picking

        def loading(kind, name):
            module = load(kind, name)
            if (kind, name) == ("drivers", "serve_lm"):
                module._pick = of_the_first(module._pick)
            return module

        ctx.load = loading
        return ctx
    return steady
