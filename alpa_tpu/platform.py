"""Process-level backend helpers.

Tests and the multi-chip dry run need an n-device virtual CPU backend;
``pin_cpu_platform`` is the single place that knows how to force it (used
by ``tests/conftest.py`` and ``__graft_entry__.dryrun_multichip``).
Entry points that run on the chip call ``enable_compilation_cache`` so
that compiled programs persist between processes.
"""
import os


def set_cpu_device_count(n_devices: int) -> None:
    """Request ``n_devices`` virtual CPU devices WITHOUT touching the
    backend (multi-process workers must still run
    ``jax.distributed.initialize`` afterwards, which a backend probe
    would break).  Must run before the first jax backend use."""
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError as e:
        raise RuntimeError(
            "CPU pin ineffective — a jax backend was already initialized "
            "in this process; call pin_cpu_platform() before any jax "
            "operation, or run in a fresh process") from e


def pin_cpu_platform(n_devices: int) -> None:
    """Pin this process to an ``n_devices``-device virtual CPU backend.

    Must run before the first jax backend use.  Mutates process-global
    jax config and initializes the backend to verify the pin took: any
    later work in the same process sees the CPU backend — run TPU work
    in a separate process.
    """
    import jax

    set_cpu_device_count(n_devices)
    devices = jax.devices()
    assert devices[0].platform == "cpu" and len(devices) == n_devices, (
        f"expected {n_devices} cpu devices, got {devices}")


#: where the compile cache lives when the environment names no place:
#: one fixed path in the checkout (the path is part of the cache key)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it
    itself and no other directory is set here; where it is not, the
    cache lands at ``DEFAULT_COMPILATION_CACHE_DIR``.  Called by entry
    points (``chip_smoke.py``, ``chipbench/run.py``, the examples),
    never by ``import alpa_tpu``.  This is jax's cache of
    compiled programs; ``ALPA_TPU_CACHE_DIR`` (the plan cache) is a
    different thing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILATION_CACHE_DIR)
    return DEFAULT_COMPILATION_CACHE_DIR
