"""Host clock around making the weights (and, in training, the optimizer
state) on the device, ended by ``block_until_ready``."""


def read(obs):
    return obs["timers"].get("state_init_s")
