"""Seconds from the start of the process to the first measured step or
request: imports, planning, compilation (or reading the compile cache),
making the weights, the reference's loss where it runs first, warm-up."""


def read(obs):
    return obs["setup_s"]
