"""Seconds jax spent in the backend compiler (or reading a compiled program
back from the persistent cache), summed over every program of the run, from
``jax.monitoring``'s ``backend_compile_duration``."""


def read(obs):
    return obs["compile"]["seconds"].get(
        "/jax/core/compile/backend_compile_duration")
